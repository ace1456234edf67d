"""Resilient compute plane (ISSUE 5): CircuitBreaker state machine,
BackendHealthGovernor shadow verification / quarantine / probed
recovery, the Fib agent breaker, and the 9-node ``tpu_corrupt`` chaos
acceptance run — silent device corruption is DETECTED (RIB diff against
the scalar oracle), the device is QUARANTINED (route builds, serving and
what-if degrade coherently), routes keep flowing from the scalar engine
with invariants green, and a half-open probe RESTORES the device after
heal — all deterministic from one seed.
"""

import asyncio
import dataclasses
import math

import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.config import ResilienceConfig
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, ring_edges
from openr_tpu.resilience import (
    STATE_CLOSED,
    STATE_HALF_OPEN,
    STATE_OPEN,
    CircuitBreaker,
)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# CircuitBreaker: SimClock-deterministic state machine
# ---------------------------------------------------------------------------


def make_breaker(clock, **kw):
    kw.setdefault("failure_threshold", 3)
    kw.setdefault("backoff_initial_s", 1.0)
    kw.setdefault("backoff_max_s", 8.0)
    kw.setdefault("jitter_pct", 0.0)
    return CircuitBreaker("test", clock, **kw)


def test_breaker_closed_to_open_to_half_open_to_closed():
    clock = SimClock()
    br = make_breaker(clock)
    assert br.state == STATE_CLOSED and br.allow_request()
    br.record_failure()
    br.record_failure()
    assert br.state == STATE_CLOSED  # below threshold
    br.record_failure()
    assert br.state == STATE_OPEN and br.num_opens == 1
    assert not br.allow_request()  # hold not elapsed -> short-circuit
    assert br.num_short_circuits == 1
    clock._now += 1.5  # past the 1s hold
    assert br.allow_request()  # THE probe
    assert br.state == STATE_HALF_OPEN and br.num_probes == 1
    br.record_success()
    assert br.state == STATE_CLOSED and br.num_closes == 1
    # the ladder reset: a fresh failure run re-opens at the initial hold
    for _ in range(3):
        br.record_failure()
    assert br.state == STATE_OPEN and br.current_hold_s() == 1.0


def test_breaker_failed_probe_doubles_the_hold():
    clock = SimClock()
    br = make_breaker(clock)
    for _ in range(3):
        br.record_failure()
    assert br.current_hold_s() == 1.0
    clock._now += 2.0
    assert br.allow_request()
    br.record_failure()  # probe failed
    assert br.state == STATE_OPEN
    assert br.num_probe_failures == 1
    assert br.current_hold_s() == 2.0  # doubled
    clock._now += 3.0
    assert br.allow_request()
    br.record_failure()
    assert br.current_hold_s() == 4.0
    # ...capped at the max
    for _ in range(4):
        clock._now += 100.0
        assert br.allow_request()
        br.record_failure()
    assert br.current_hold_s() == 8.0


def test_breaker_concurrent_probe_exclusion():
    clock = SimClock()
    br = make_breaker(clock)
    for _ in range(3):
        br.record_failure()
    clock._now += 2.0
    assert br.allow_request()  # probe owner
    # everyone else is short-circuited until the probe resolves
    assert not br.allow_request()
    assert not br.allow_request()
    br.record_success()
    assert br.allow_request()  # closed again


def test_breaker_probe_exclusion_under_concurrent_callers():
    """Two ACTORS racing a half-open breaker (satellite, ISSUE 6): both
    wake at the same virtual instant once the hold elapses; exactly one
    wins the probe slot, the loser short-circuits — deterministically
    under SimClock (wake order is the deterministic sleep-registration
    order, so replays are byte-identical)."""

    async def main():
        clock = SimClock()
        br = make_breaker(clock)
        for _ in range(3):
            br.record_failure()
        assert br.state == STATE_OPEN
        outcomes = {}

        async def caller(name):
            await clock.sleep(2.0)  # both due at the same virtual time
            outcomes[name] = br.allow_request()

        t1 = asyncio.ensure_future(caller("a"))
        t2 = asyncio.ensure_future(caller("b"))
        await clock.run_for(3.0)
        await asyncio.gather(t1, t2)
        # exactly ONE probe admitted; the loser short-circuited
        assert sorted(outcomes.values()) == [False, True]
        assert br.state == STATE_HALF_OPEN
        assert br.num_probes == 1 and br.num_short_circuits == 1
        # deterministic winner: sleep-registration order
        assert outcomes["a"] is True and outcomes["b"] is False
        # the probe resolves; admission reopens for everyone
        br.record_success()
        assert br.allow_request()

    run(main())


def test_breaker_release_probe_is_unscored():
    clock = SimClock()
    br = make_breaker(clock)
    br.force_open()
    clock._now += 2.0
    assert br.allow_request()
    hold = br.current_hold_s()
    br.release_probe()  # probe never exercised the dependency
    assert br.state == STATE_OPEN
    assert br.num_probe_failures == 0
    assert br.current_hold_s() == hold  # no escalation
    assert br.allow_request()  # immediately re-probeable


def test_breaker_jitter_bounds_and_determinism():
    def holds(seed):
        clock = SimClock()
        br = make_breaker(clock, jitter_pct=0.2, seed=seed)
        out = []
        for _ in range(6):
            br.force_open()
            out.append(br.current_hold_s())
            br.force_close()
        return out

    a = holds(5)
    # every draw within +/- jitter of the 1s base, and actually jittered
    assert all(0.8 <= h <= 1.2 for h in a), a
    assert len(set(a)) > 1, "jitter must vary across draws"
    # deterministic from the seed (the chaos reproducibility contract)
    assert a == holds(5)
    assert a != holds(6)


# ---------------------------------------------------------------------------
# BackendHealthGovernor over a real TpuBackend (small ring LSDB)
# ---------------------------------------------------------------------------


def make_world(n=6):
    edges = ring_edges(n)
    ls = LinkState("0", "node0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    from openr_tpu.types import PrefixEntry

    for i in range(n):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.7.{i}.0/24"))
    return {"0": ls}, ps


def make_backend(clock, **resilience_kw):
    from openr_tpu.decision.backend import TpuBackend

    resilience_kw.setdefault("shadow_sample_every", 1)
    resilience_kw.setdefault("failure_threshold", 2)
    resilience_kw.setdefault("probe_backoff_initial_s", 1.0)
    resilience_kw.setdefault("probe_backoff_max_s", 8.0)
    resilience_kw.setdefault("jitter_pct", 0.0)
    return TpuBackend(
        SpfSolver("node0"),
        clock=clock,
        resilience=ResilienceConfig(**resilience_kw),
    )


def norm_db(db):
    return {
        p: (sorted((nh.neighbor_node_name, nh.metric) for nh in e.nexthops),
            float(e.igp_cost))
        for p, e in db.unicast_routes.items()
    }


def test_shadow_verification_passes_on_healthy_device():
    als, ps = make_world()
    backend = make_backend(SimClock())
    db = backend.build_route_db(als, ps)
    gov = backend.governor
    assert gov.num_shadow_checks >= 1
    assert gov.num_shadow_mismatches == 0
    assert not backend.device_failed
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))


def test_sdc_detected_quarantined_and_served_from_scalar():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    backend.build_route_db(als, ps)  # healthy baseline build
    backend.inject_silent_corruption(True)
    db = backend.build_route_db(als, ps, force_full=True)
    gov = backend.governor
    # detected on the sampled build, quarantined, and THE RETURNED DB IS
    # THE SCALAR ORACLE'S — the corrupt answer never leaves the backend
    assert gov.num_shadow_mismatches == 1
    assert gov.num_quarantines == 1
    assert backend.device_failed
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    # while quarantined: scalar fallbacks, the device is never touched
    before = backend.num_device_builds
    db2 = backend.build_route_db(als, ps)
    assert backend.num_device_builds == before
    assert backend.num_fallback_injected >= 1
    assert norm_db(db2) == norm_db(db)


def test_shadow_check_counts_the_oracles_nexthop_memo():
    """Each shadow rebuild adds its next-hop memo's hits and misses to
    the governor's counters; the memo changes no verdict: corruption is
    still caught and the scalar RouteDb served."""
    from openr_tpu.types import PrefixEntry

    als, ps = make_world()
    for i in range(6):  # three prefixes behind each node's one winner set
        for p in (1, 2):
            ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.8.{i}.{p}/32"))
    backend = make_backend(SimClock())
    gov = backend.governor
    hits_key = "resilience.backend.shadow_nexthop_memo_hits"
    misses_key = "resilience.backend.shadow_nexthop_memo_misses"
    backend.build_route_db(als, ps)
    checks = gov.num_shadow_checks
    assert checks >= 1
    # per rebuild: 5 remote winner sets, 15 remote prefixes
    assert gov.counters.get(misses_key) == 5 * checks
    assert gov.counters.get(hits_key) == 10 * checks
    snap = gov.counter_snapshot()
    assert snap[hits_key] == 10 * checks and snap[misses_key] == 5 * checks

    backend.inject_silent_corruption(True)
    db = backend.build_route_db(als, ps, force_full=True)
    assert gov.num_shadow_checks == checks + 1
    assert gov.num_shadow_mismatches == 1 and backend.device_failed
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert gov.counters.get(misses_key) == 5 * (checks + 1)
    assert gov.counters.get(hits_key) == 10 * (checks + 1)


def test_probed_recovery_after_corruption_heals():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    gov = backend.governor
    backend.build_route_db(als, ps)
    backend.inject_silent_corruption(True)
    backend.build_route_db(als, ps, force_full=True)
    assert backend.device_failed
    # heal the kernel, but the hold hasn't elapsed: still scalar
    backend.inject_silent_corruption(False)
    backend.build_route_db(als, ps)
    assert backend.device_failed
    # hold elapses -> the next build is the half-open probe; it passes
    # shadow verification and restores the device
    clock._now += 5.0
    db = backend.build_route_db(als, ps, force_full=True)
    assert not backend.device_failed
    assert gov.num_restores == 1
    assert gov.breaker.num_probes >= 1
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))


def test_failed_probe_reopens_with_doubled_hold():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    gov = backend.governor
    backend.build_route_db(als, ps)
    backend.inject_silent_corruption(True)
    backend.build_route_db(als, ps, force_full=True)
    hold0 = gov.breaker.current_hold_s()
    clock._now += hold0 + 0.5
    # still corrupt: the probe build FAILS verification -> re-quarantine
    backend.build_route_db(als, ps, force_full=True)
    assert backend.device_failed
    assert gov.breaker.num_probe_failures == 1
    assert gov.breaker.current_hold_s() == 2 * hold0


def test_dispatch_failures_trip_the_latch_after_threshold():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock, failure_threshold=2)
    gov = backend.governor
    oracle = norm_db(SpfSolver("node0").build_route_db(als, ps))
    orig = backend._build_device

    def explode(*a, **k):
        raise RuntimeError("chip fell over")

    backend._build_device = explode
    # failure 1: scalar fallback for this build, latch still down
    db1 = backend.build_route_db(als, ps)
    assert norm_db(db1) == oracle
    assert not backend.device_failed and backend.num_dispatch_errors == 1
    # failure 2: threshold reached -> quarantined (no more re-paying the
    # failing device on every rebuild)
    db2 = backend.build_route_db(als, ps)
    assert norm_db(db2) == oracle
    assert backend.device_failed and gov.num_quarantines == 1
    touched = []
    backend._build_device = lambda *a, **k: touched.append(1)
    backend.build_route_db(als, ps)
    assert not touched, "quarantined build must not touch the device"
    # device heals; the hold elapses; the probe restores
    backend._build_device = orig
    clock._now += 10.0
    db3 = backend.build_route_db(als, ps, force_full=True)
    assert not backend.device_failed
    assert norm_db(db3) == oracle


@pytest.mark.parametrize(
    "exc, scored",
    [
        ("capacity", False),  # the repo's own data-scale limit
        ("value", True),  # jaxlib's XLA errors and native faults
    ],
)
def test_only_capacity_errors_skip_the_breaker(exc, scored):
    """A CapacityError is a data limit: scalar fallback, breaker
    untouched.  Any other ValueError (jaxlib surfaces XLA errors as
    ValueError; the native CSR fill reports a fault so) is a dispatch
    failure the breaker counts — never a quiet scalar build."""
    from openr_tpu.ops.csr import CapacityError

    als, ps = make_world()
    backend = make_backend(SimClock())
    oracle = norm_db(SpfSolver("node0").build_route_db(als, ps))

    def explode(*a, **k):
        if exc == "capacity":
            raise CapacityError("9 exceeds largest bucket 8")
        raise ValueError("INVALID_ARGUMENT: XLA refused the launch")

    backend._build_device = explode
    assert norm_db(backend.build_route_db(als, ps)) == oracle
    assert backend.num_dispatch_errors == int(scored)
    assert backend.governor.num_dispatch_failures == int(scored)
    assert backend.num_scalar_builds == 1


def test_non_finite_guard_trips_shadow_verification():
    als, ps = make_world()
    backend = make_backend(SimClock())
    gov = backend.governor
    db = SpfSolver("node0").build_route_db(als, ps)
    prefix, entry = next(iter(db.unicast_routes.items()))
    db.unicast_routes[prefix] = dataclasses.replace(
        entry, igp_cost=float("nan")
    )
    ok, scalar_db, reason = gov._shadow_verify(db, als, ps)
    assert not ok and reason.startswith("non_finite")
    assert scalar_db is not None
    assert all(
        math.isfinite(e.igp_cost)
        for e in scalar_db.unicast_routes.values()
    )


def test_hard_quarantine_blocks_probes_until_requested():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    gov = backend.governor
    backend.build_route_db(als, ps)
    gov.force_quarantine(reason="chaos")
    assert backend.device_failed and gov.injected
    # injected outage: NO probes, however long the clock runs — the
    # fault owner declared the device dead
    clock._now += 500.0
    before = backend.num_device_builds
    backend.build_route_db(als, ps)
    assert backend.num_device_builds == before and backend.device_failed
    # the heal is PROBED: request_probe makes the next build a verified
    # probe solve, which restores
    gov.request_probe(reason="chaos_heal")
    assert backend.device_failed  # not restored until the probe passes
    backend.build_route_db(als, ps, force_full=True)
    assert not backend.device_failed and gov.num_restores == 1


def test_forced_probe_mismatch_quarantines_even_from_closed():
    """An operator `force_probe` that catches corruption must quarantine
    outright — even with sampling disabled and the breaker closed
    (probes ALWAYS shadow-verify; proven corruption is never ignored)."""
    als, ps = make_world()
    backend = make_backend(SimClock(), shadow_sample_every=0)
    gov = backend.governor
    backend.build_route_db(als, ps)
    assert gov.num_shadow_checks == 0  # sampling off: no routine checks
    backend.inject_silent_corruption(True)
    backend.build_route_db(als, ps, force_full=True)
    assert not backend.device_failed  # unsampled corruption undetected...
    out = gov.probe_now(als, ps)  # ...until the operator probes
    assert out["probed"] and out["passed"] is False
    assert backend.device_failed and gov.num_quarantines == 1


def test_operator_probe_now_restores_a_quarantined_device():
    als, ps = make_world()
    backend = make_backend(SimClock())
    gov = backend.governor
    backend.build_route_db(als, ps)
    gov.force_quarantine(reason="operator")
    out = gov.probe_now(als, ps)
    assert out["probed"] and out["passed"] and out["restored"]
    assert not backend.device_failed
    # with no LSDB there is nothing to probe against
    assert gov.probe_now({}, PrefixState())["probed"] is False


# ---------------------------------------------------------------------------
# Fib agent breaker: short-circuit while open, probe-close on retry
# ---------------------------------------------------------------------------


def test_fib_breaker_short_circuits_and_recovers():
    from openr_tpu.config import FibConfig
    from openr_tpu.decision.rib import (
        DecisionRouteUpdate,
        DecisionRouteUpdateType,
        RibUnicastEntry,
    )
    from openr_tpu.fib.fib import Fib, MockFibAgent
    from openr_tpu.messaging.queue import ReplicateQueue
    from openr_tpu.types import NextHop

    def route(prefix):
        return RibUnicastEntry(
            prefix=prefix,
            nexthops={NextHop(address="fe80::1", if_name="if1")},
        )

    async def main():
        clock = SimClock()
        q = ReplicateQueue("routeUpdates")
        agent = MockFibAgent(clock)
        fib = Fib(
            node_name="me",
            clock=clock,
            config=FibConfig(),
            agent=agent,
            route_updates_reader=q.get_reader(),
        )
        fib.start()
        q.push(
            DecisionRouteUpdate(
                type=DecisionRouteUpdateType.FULL_SYNC,
                unicast_routes_to_update={"10.0.0.0/24": route("10.0.0.0/24")},
            )
        )
        await clock.run_for(1.0)
        assert fib.breaker.state == STATE_CLOSED
        agent.fail = True
        q.push(
            DecisionRouteUpdate(
                unicast_routes_to_update={"10.1.0.0/24": route("10.1.0.0/24")}
            )
        )
        await clock.run_for(0.001)
        # first failure opened the breaker (threshold 1)
        assert fib.breaker.state != STATE_CLOSED and fib._dirty
        # further incremental updates SHORT-CIRCUIT: the failing agent is
        # not paid another per-update RPC (adds counter is frozen)
        adds_before = agent.num_add
        q.push(
            DecisionRouteUpdate(
                unicast_routes_to_update={"10.2.0.0/24": route("10.2.0.0/24")}
            )
        )
        await clock.run_for(0.001)
        assert agent.num_add == adds_before
        assert fib.breaker.num_short_circuits >= 1
        # desired state still tracked; agent heals; retry probes close it
        agent.fail = False
        await clock.run_for(30.0)
        assert not fib._dirty and fib.breaker.state == STATE_CLOSED
        assert "10.2.0.0/24" in agent.unicast
        gauges = fib.retry_state()
        assert gauges["resilience.fib_agent.state"] == 0.0
        assert gauges["resilience.fib_agent.opens"] >= 1
        await fib.stop()

    run(main())


# ---------------------------------------------------------------------------
# 9-node tpu_corrupt chaos acceptance: detect -> quarantine -> scalar
# serve -> probed recovery, deterministic from one seed
# ---------------------------------------------------------------------------

VICTIM = "node4"
SAMPLE_EVERY = 2


def corrupt_overrides(cfg):
    cfg.watchdog_config.interval_s = 1.0
    # always-device: the 9-node grid must actually exercise the kernel
    cfg.tpu_compute_config.min_device_prefixes = 0
    cfg.resilience_config = ResilienceConfig(
        shadow_sample_every=SAMPLE_EVERY,
        failure_threshold=2,
        probe_backoff_initial_s=0.5,
        probe_backoff_max_s=4.0,
        jitter_pct=0.1,
        seed=7,
    )


async def _corrupt_run():
    from openr_tpu.chaos import ChaosController, FaultPlan, InvariantChecker
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges
    from openr_tpu.types import PrefixEntry

    clock = SimClock()
    net = EmulatedNetwork(
        clock, use_tpu_backend=True, config_overrides=corrupt_overrides
    )
    net.build(grid_edges(3))  # 9 nodes
    net.start()
    checker = InvariantChecker(net)
    plan = FaultPlan().tpu_corrupt(VICTIM, at=2.0, duration=10.0)
    controller = ChaosController(net, plan, seed=7)

    await clock.run_for(18.0)
    ok, why = net.converged_full_mesh()
    assert ok, why
    victim = net.nodes[VICTIM]
    gov = victim.decision.backend.governor
    assert gov is not None and not gov.quarantined

    controller.start()
    await clock.run_for(3.0)  # corruption live at t=2
    # drive rebuilds during the corrupt window: each advertisement floods
    # to every node and triggers a (corrupted, on the victim) device
    # build; detection must land within ONE shadow-sample interval
    for i in range(SAMPLE_EVERY):
        net.nodes["node0"].advertise_prefixes(
            [PrefixEntry(f"10.99.{i}.0/24")]
        )
        await clock.run_for(1.5)
        checker.sample()
    assert gov.num_shadow_mismatches >= 1, (
        "silent corruption escaped shadow verification"
    )
    assert gov.quarantined and victim.decision.backend.device_failed
    # availability degrades COHERENTLY: serving/what-if gate on the same
    # latch route builds do
    assert not victim.decision.device_available()
    # ...and the victim's FIB is still exact (scalar engine serving):
    # its routes match a fresh scalar oracle of its own vantage, and no
    # blackholes anywhere
    checker.check_no_blackholes()
    oracle = SpfSolver(VICTIM).build_route_db(
        victim.decision.area_link_states, victim.decision.prefix_state
    )
    assert norm_db(victim.decision.route_db) == norm_db(oracle)

    # heal fires at t=12 (chaos routes it through the governor: the next
    # build is a probe); drive one more rebuild to carry the probe
    await clock.run_for(8.0)
    net.nodes["node0"].advertise_prefixes([PrefixEntry("10.99.8.0/24")])
    await clock.run_for(4.0)
    assert not gov.quarantined, "device not restored after heal + probe"
    assert victim.decision.device_available()
    assert gov.num_restores >= 1
    assert gov.breaker.num_probes >= 1

    await clock.run_for(8.0)
    checker.check_all()  # LSDB converged, FIBs blackhole-free, full mesh
    assert controller.done

    chaos_dump = controller.counter_dump()
    resilience_dump = victim.counters.dump("resilience.")
    assert resilience_dump.get("resilience.backend.shadow_mismatches", 0) >= 1
    await controller.stop()
    await net.stop()
    return chaos_dump, resilience_dump


@pytest.mark.chaos
def test_tpu_corrupt_detect_quarantine_recover_deterministic():
    a = run(_corrupt_run())
    b = run(_corrupt_run())
    # reproducibility contract: same seed => byte-identical dumps
    assert a == b
    chaos_dump, _ = a
    assert chaos_dump["chaos.injects"] == 1
    assert chaos_dump["chaos.heals"] == 1
    assert "chaos.inject.tpu_corrupt.node4" in chaos_dump


async def _warm_purge_run():
    """ISSUE-9 purge semantics under chaos: ``tpu_corrupt`` landing
    DURING a warm-rebuild regime invalidates the warm context — the
    next device build is cold AND scalar-verified — and warm rebuilds
    resume after probed recovery.  Returns the counters a replay must
    reproduce byte-identically."""
    from openr_tpu.chaos import ChaosController, FaultPlan, InvariantChecker
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges
    from openr_tpu.types import PrefixEntry

    clock = SimClock()
    net = EmulatedNetwork(
        clock, use_tpu_backend=True, config_overrides=corrupt_overrides
    )
    net.build(grid_edges(3))
    net.start()
    checker = InvariantChecker(net)
    plan = FaultPlan().tpu_corrupt(VICTIM, at=8.0, duration=10.0)
    controller = ChaosController(net, plan, seed=13)

    await clock.run_for(18.0)
    ok, why = net.converged_full_mesh()
    assert ok, why
    victim = net.nodes[VICTIM]
    backend = victim.decision.backend
    gov = backend.governor
    # a link flap before the fault: a warm-classified perturbation tick
    # — the warm rebuild engages and flows through shadow verification
    # like any other build (sample_every=2 on a warm regime)
    controller.start()  # fault fires at t=+8
    net.fail_link("node0", "node1")
    await clock.run_for(3.0)
    net.restore_link("node0", "node1")
    await clock.run_for(3.0)
    warm_before_fault = backend.num_warm_builds
    assert warm_before_fault >= 1, "perturbation ticks must warm-rebuild"
    assert gov.num_shadow_mismatches == 0
    await clock.run_for(3.0)  # corruption live at t=+8
    # the injection purged the warm context immediately
    assert backend.gen.warm_ctx is None
    assert backend._warm_purge_reasons.get("tpu_corrupt", 0) >= 1
    purges_at_fault = backend.num_warm_purges
    # drive a rebuild during the corrupt window: the purge armed a
    # forced shadow check, so the FIRST corrupt device build is caught
    net.nodes["node0"].advertise_prefixes([PrefixEntry("10.98.0.0/24")])
    await clock.run_for(1.5)
    checker.sample()
    assert gov.num_shadow_mismatches >= 1
    assert gov.quarantined
    checker.check_no_blackholes()
    # heal at t=+18; probe restores; a fresh perturbation warms again
    await clock.run_for(12.0)
    net.nodes["node0"].advertise_prefixes([PrefixEntry("10.98.1.0/24")])
    await clock.run_for(4.0)
    assert not gov.quarantined
    net.fail_link("node1", "node2")
    await clock.run_for(4.0)
    # the first post-purge device build re-solved cold and
    # re-established the context; by now warm rebuilds have resumed
    assert backend.num_warm_builds > warm_before_fault
    assert backend.gen.warm_ctx is not None
    await clock.run_for(6.0)
    checker.check_all()
    stats = (
        backend.num_warm_builds,
        backend.num_warm_purges - purges_at_fault,
        sorted(backend._warm_purge_reasons.items()),
        sorted(backend._warm_fallback_reasons.items()),
        gov.num_shadow_mismatches,
    )
    dumps = (
        controller.counter_dump(),
        victim.counters.dump("resilience."),
        stats,
    )
    await controller.stop()
    await net.stop()
    return dumps


@pytest.mark.chaos
def test_tpu_corrupt_purges_warm_context_deterministic():
    a = run(_warm_purge_run())
    b = run(_warm_purge_run())
    assert a == b  # byte-identical seeded replay (ISSUE-9 acceptance)


@pytest.mark.chaos
def test_tpu_corrupt_on_scalar_backend_is_a_counted_noop():
    from openr_tpu.chaos import ChaosController, FaultPlan
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import line_edges

    async def main():
        clock = SimClock()
        net = EmulatedNetwork(clock)  # scalar backends
        net.build(line_edges(2))
        net.start()
        plan = FaultPlan().tpu_corrupt("node0", at=0.0, duration=1.0)
        controller = ChaosController(net, plan, seed=1)
        await clock.run_for(5.0)
        controller.start()
        await clock.run_for(5.0)
        dump = controller.counter_dump()
        assert dump["chaos.tpu_corrupt.noop"] == 2  # inject + heal
        await controller.stop()
        await net.stop()

    run(main())
