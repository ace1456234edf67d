"""Per-device mesh health governance (ISSUE 6): the DevicePool shard
plane, per-chip shadow attribution — ONE lying chip is quarantined
individually, its shard re-packs onto the survivors, and the node keeps
serving — plus per-chip probed recovery and the 9-node emulation
acceptance with a ``tpu_corrupt(node, device_index=k)`` chaos fault,
deterministic from one seed.
"""

import asyncio

import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.config import ParallelConfig, ResilienceConfig
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, ring_edges
from openr_tpu.parallel.mesh import DevicePool, make_mesh
from openr_tpu.types import PrefixEntry

pytestmark = pytest.mark.multichip


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# DevicePool + make_mesh validation (satellite)
# ---------------------------------------------------------------------------


def test_make_mesh_validates_and_pins_devices():
    import jax

    devices = jax.devices()
    assert len(devices) == 8  # the conftest's forced virtual mesh
    with pytest.raises(ValueError, match="only 8"):
        make_mesh(9)
    with pytest.raises(ValueError, match=">= 1"):
        make_mesh(0)
    # explicit devices= pins placement (survivor meshes, tests)
    mesh = make_mesh(devices=[devices[3], devices[5]])
    assert list(mesh.devices.flat) == [devices[3], devices[5]]
    with pytest.raises(ValueError, match="contradicts"):
        make_mesh(3, devices=devices[:2])
    with pytest.raises(ValueError, match="at least one"):
        make_mesh(devices=[])


def test_device_pool_shard_packing_and_health():
    pool = DevicePool()
    assert pool.size == 8 and pool.num_healthy == 8
    with pytest.raises(ValueError):
        DevicePool(max_devices=99)
    # even contiguous packing, remainder on the leading shards
    assert pool.shard_ranges(10) == [
        (0, 0, 2), (1, 2, 4), (2, 4, 5), (3, 5, 6), (4, 6, 7),
        (5, 7, 8), (6, 8, 9), (7, 9, 10),
    ]
    # devices that would get zero rows are dropped
    assert pool.shard_ranges(3) == [(0, 0, 1), (1, 1, 2), (2, 2, 3)]
    # quarantine re-packs onto survivors deterministically
    assert pool.quarantine_device(2)
    assert not pool.quarantine_device(2)  # idempotent
    assert pool.num_healthy == 7 and pool.lead_index() == 0
    assert 2 not in [d for d, _lo, _hi in pool.shard_ranges(14)]
    assert pool.restore_device(2)
    assert pool.healthy_mask() == [True] * 8
    assert pool.num_quarantines == 1 and pool.num_restores == 1


def test_device_pool_survivor_mesh_spans_healthy_chips():
    pool = DevicePool()
    assert pool.survivor_mesh().devices.size == 8
    pool.quarantine_device(1)
    assert pool.survivor_mesh().devices.size == 7


# ---------------------------------------------------------------------------
# TpuBackend per-chip governance (small ring LSDB, forced sharding)
# ---------------------------------------------------------------------------


def make_world(n=6):
    ls = LinkState("0", "node0")
    for db in build_adj_dbs(ring_edges(n)).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.7.{i}.0/24"))
    return {"0": ls}, ps


def make_backend(clock, **kw):
    from openr_tpu.decision.backend import TpuBackend

    kw.setdefault("shadow_sample_every", 1)
    kw.setdefault("failure_threshold", 2)
    kw.setdefault("probe_backoff_initial_s", 1.0)
    kw.setdefault("probe_backoff_max_s", 8.0)
    kw.setdefault("jitter_pct", 0.0)
    return TpuBackend(
        SpfSolver("node0"),
        clock=clock,
        resilience=ResilienceConfig(**kw),
        # min_shard_rows=0: the tiny test world must actually shard
        # across the 8-chip pool so per-chip attribution is exercised
        parallel=ParallelConfig(min_shard_rows=0),
    )


def norm_db(db):
    return {
        p: (sorted((nh.neighbor_node_name, nh.metric) for nh in e.nexthops),
            float(e.igp_cost))
        for p, e in db.unicast_routes.items()
    }


def test_full_build_shards_across_the_pool_with_parity():
    als, ps = make_world()
    backend = make_backend(SimClock())
    db = backend.build_route_db(als, ps)
    assert backend.gen.attr_plan is not None
    devs = [d for d, _lo, _hi in backend.gen.attr_plan]
    assert len(devs) > 1, "tiny world must still shard (min_shard_rows=0)"
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))


def test_mid_stream_chip_failure_quarantines_and_recovery_is_probed():
    """ISSUE-11 satellite: a shard failing at streamed drain time
    quarantines ITS chip via ``governor.record_stream_failure`` (unlike
    the old unattributable barrier raise, which scored the WHOLE-backend
    breaker), the build re-packs its exact row range onto survivors with
    no rows dropped or duplicated, and the chip earns its way back
    through the normal per-chip half-open probe cycle — no fault owner
    heal needed."""
    clock = SimClock()
    als, ps = make_world()
    backend = make_backend(clock)
    fired = []

    def fault(dev_index):
        if dev_index == 2 and not fired:
            fired.append(dev_index)
            raise RuntimeError("injected stream failure")

    backend._stream_fault = fault
    db = backend.build_route_db(als, ps)
    assert fired == [2]
    assert backend.num_stream_repacks == 1
    assert not backend.pool.is_healthy(2)
    assert norm_db(db) == norm_db(
        SpfSolver("node0").build_route_db(als, ps)
    )
    backend._stream_fault = None
    # the next build excludes the chip and stays correct
    db2 = backend.build_route_db(als, ps)
    assert 2 not in {d for d, _lo, _hi in (backend.gen.attr_plan or ())}
    assert norm_db(db2) == norm_db(
        SpfSolver("node0").build_route_db(als, ps)
    )
    # after the breaker hold elapses, the chip probes back in on its
    # own (NOT injected-latched like chaos tpu_fail) and is restored
    clock._now += 60.0
    for _ in range(4):
        backend.build_route_db(als, ps)
        clock._now += 60.0
    assert backend.pool.is_healthy(2)


def test_one_corrupt_chip_is_quarantined_individually():
    als, ps = make_world()
    backend = make_backend(SimClock())
    gov = backend.governor
    oracle = norm_db(SpfSolver("node0").build_route_db(als, ps))
    backend.build_route_db(als, ps)
    backend.inject_silent_corruption(True, device_index=3)
    db = backend.build_route_db(als, ps, force_full=True)
    # detected on the sampled build; ONLY chip 3 quarantined; the
    # verified scalar answer is served; the node-level latch stays DOWN
    assert gov.num_shadow_mismatches == 1
    assert gov.num_chip_quarantines == 1 and gov.num_quarantines == 0
    assert not backend.device_failed
    assert backend.pool.healthy_mask() == [
        True, True, True, False, True, True, True, True
    ]
    assert norm_db(db) == oracle
    # the quarantine swap forces a whole-RIB diff (corrupt-entry purge)
    assert backend.take_full_replace()
    # survivors keep serving: the next build re-packs without chip 3
    db2 = backend.build_route_db(als, ps, force_full=True)
    assert 3 not in [d for d, _lo, _hi in backend.gen.attr_plan]
    assert norm_db(db2) == oracle


def test_device_scoped_corrupt_purges_warm_context_and_repacks():
    """ISSUE-9 purge semantics, per-chip scope: a ``tpu_corrupt``
    targeting ONE chip during a warm-rebuild regime invalidates the
    warm context, the next build is cold and scalar-verified (catching
    the lying chip, which quarantines INDIVIDUALLY while its shard
    re-packs), and warm rebuilds resume on the survivors — with the
    quarantined chip's stale table replica dropped."""
    from openr_tpu.emulation.topology import build_adj_dbs as _adj

    adj = _adj(ring_edges(6))
    ls = LinkState("0", "node0")
    for db in adj.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(6):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.7.{i}.0/24"))
    als = {"0": ls}
    backend = make_backend(SimClock(), shadow_sample_every=100)
    gov = backend.governor

    def perturb(metric):
        db = adj["node3"]
        db.adjacencies[0].metric = metric
        ls.update_adjacency_database(db)

    backend.build_route_db(als, ps)  # first build (verified, cold)
    perturb(2)
    backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert backend.num_warm_builds == 1
    assert backend.gen.warm_ctx is not None
    # chip-scoped corruption: warm context purged IMMEDIATELY, and the
    # purge arms a forced shadow check for the next device build
    backend.inject_silent_corruption(True, device_index=3)
    assert backend.gen.warm_ctx is None
    assert backend.num_warm_purges == 1
    db = backend.build_route_db(als, ps, force_full=True)
    assert gov.num_shadow_mismatches == 1
    assert gov.num_chip_quarantines == 1 and not backend.device_failed
    assert not backend.pool.is_healthy(3)
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    backend.inject_silent_corruption(False, device_index=3)
    # next perturbation: cold (context purged; the quarantine listener
    # purged again — idempotent), then the re-established context warms
    perturb(3)
    backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert backend.num_warm_builds == 1
    assert backend._warm_fallback_reasons.get("no_context", 0) >= 1
    # the re-pack dropped the quarantined chip's table replica
    assert 3 not in backend.gen.spf_replicas
    perturb(4)
    db = backend.build_route_db(
        als, ps, changed_prefixes=set(), force_full=True, warm_delta=True
    )
    assert backend.num_warm_builds == 2
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    assert backend._warm_purge_reasons.get("tpu_corrupt", 0) >= 1
    assert backend._warm_purge_reasons.get("quarantine", 0) >= 1


def test_chip_probe_spans_carry_the_device_attr():
    """`resilience.probe` spans gain a `device` attr (ISSUE 6 tracing
    surface): per-chip probes are distinguishable in a trace."""
    from openr_tpu.tracing import Tracer

    als, ps = make_world()
    clock = SimClock()
    tracer = Tracer("node0", clock=clock)
    from openr_tpu.decision.backend import TpuBackend

    backend = TpuBackend(
        SpfSolver("node0"),
        clock=clock,
        tracer=tracer,
        resilience=ResilienceConfig(shadow_sample_every=1, jitter_pct=0.0),
        parallel=ParallelConfig(min_shard_rows=0),
    )
    gov = backend.governor
    backend.build_route_db(als, ps)
    gov.force_quarantine_device(4, reason="drill")
    gov.request_probe_device(4)
    backend.build_route_db(als, ps, force_full=True)
    probes = [s for s in tracer._done if s.name == "resilience.probe"]
    assert probes, "chip probe did not record a resilience.probe span"
    assert probes[-1].attrs.get("device") == 4
    assert probes[-1].attrs.get("passed") is True


def test_failed_chip_probe_doubles_backoff_then_recovery_is_probed():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    gov = backend.governor
    oracle = norm_db(SpfSolver("node0").build_route_db(als, ps))
    backend.build_route_db(als, ps)
    backend.inject_silent_corruption(True, device_index=3)
    backend.build_route_db(als, ps, force_full=True)
    br3 = gov._chip_breaker(3)
    hold0 = br3.current_hold_s()
    # hold elapses while the chip is STILL lying: the probe shard rides
    # a survivor build, fails verification, and the backoff doubles —
    # the rest of the pool keeps serving throughout
    clock._now += hold0 + 0.5
    db = backend.build_route_db(als, ps, force_full=True)
    assert br3.num_probe_failures == 1
    assert br3.current_hold_s() == 2 * hold0
    assert not backend.pool.is_healthy(3) and not backend.device_failed
    assert norm_db(db) == oracle
    # heal: recovery happens ONLY via a shadow-verified probe on chip 3
    backend.inject_silent_corruption(False, device_index=3)
    gov.request_probe_device(3)
    db2 = backend.build_route_db(als, ps, force_full=True)
    assert backend.pool.is_healthy(3)
    assert gov.num_chip_restores == 1
    assert gov.last_probe.get("device") == 3 and gov.last_probe["passed"]
    assert norm_db(db2) == oracle


def test_chip_tpu_fail_is_injected_no_probes_until_requested():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    gov = backend.governor
    backend.build_route_db(als, ps)
    gov.force_quarantine_device(5, reason="chaos")
    assert not backend.pool.is_healthy(5) and not backend.device_failed
    # injected chip outage: NO probe shards, however long the clock runs
    clock._now += 500.0
    backend.build_route_db(als, ps, force_full=True)
    assert 5 not in [d for d, _lo, _hi in backend.gen.attr_plan]
    assert not backend.pool.is_healthy(5)
    # the heal is probed, never trusted blindly
    gov.request_probe_device(5, reason="chaos_heal")
    assert not backend.pool.is_healthy(5)
    backend.build_route_db(als, ps, force_full=True)
    assert backend.pool.is_healthy(5) and gov.num_chip_restores == 1


def test_zero_healthy_chips_is_the_degenerate_whole_device_outage():
    als, ps = make_world()
    clock = SimClock()
    backend = make_backend(clock)
    gov = backend.governor
    backend.build_route_db(als, ps)
    for k in range(backend.pool.size):
        gov.force_quarantine_device(k, reason="drain")
    # every chip out == the whole device is out: the same latch route
    # builds/serving/what-if already degrade on
    assert backend.device_failed
    before = backend.num_device_builds
    db = backend.build_route_db(als, ps)
    assert backend.num_device_builds == before  # scalar fallback
    assert norm_db(db) == norm_db(SpfSolver("node0").build_route_db(als, ps))
    # chips recover one at a time via their own probed breakers
    gov.request_probe_device(2, reason="heal")
    db2 = backend.build_route_db(als, ps, force_full=True)
    assert backend.pool.is_healthy(2)
    assert not backend.device_failed
    assert norm_db(db2) == norm_db(
        SpfSolver("node0").build_route_db(als, ps)
    )


def test_legacy_all_shard_corruption_still_trips_the_backend_latch():
    """Unattributable corruption (every exercised chip lying) keeps the
    PR-5 whole-backend semantics: scalar serve + aggregate quarantine,
    converging within a couple of sampled builds even when the batch
    was sharded."""
    als, ps = make_world()
    backend = make_backend(SimClock())
    oracle = norm_db(SpfSolver("node0").build_route_db(als, ps))
    backend.build_route_db(als, ps)
    backend.inject_silent_corruption(True)
    for _ in range(4):
        db = backend.build_route_db(als, ps, force_full=True)
        assert norm_db(db) == oracle  # the scalar answer is ALWAYS served
        if backend.device_failed:
            break
    assert backend.device_failed


def test_per_device_sdc_chaos_plan_wiring():
    """tpu_corrupt/tpu_fail carry device_index through plan + label."""
    from openr_tpu.chaos import FaultPlan

    plan = FaultPlan()
    plan.tpu_corrupt("node4", at=1.0, duration=5.0, device_index=3)
    plan.tpu_fail("node2", at=2.0, duration=5.0, device_index=1)
    labels = [f.label() for f in plan.faults]
    assert labels == ["tpu_corrupt.3.node4", "tpu_fail.1.node2"]
    # seeded sweeps draw per-chip faults only when num_devices is given
    a = FaultPlan.seeded(7, ["n0", "n1"], [("n0", "n1")], num_faults=24)
    b = FaultPlan.seeded(7, ["n0", "n1"], [("n0", "n1")], num_faults=24)
    assert a.faults == b.faults  # same seed, same plan
    c = FaultPlan.seeded(
        7, ["n0", "n1"], [("n0", "n1")], num_faults=64, num_devices=8
    )
    assert any(
        "device_index" in f.args
        for f in c.faults
        if f.kind in ("tpu_fail", "tpu_corrupt")
    )


# ---------------------------------------------------------------------------
# 9-node emulation acceptance: per-chip tpu_corrupt under chaos —
# detect -> quarantine chip k only -> survivors keep serving -> probed
# per-chip recovery, deterministic from one seed
# ---------------------------------------------------------------------------

VICTIM = "node4"
BAD_CHIP = 3
SAMPLE_EVERY = 2


def _overrides(cfg):
    cfg.watchdog_config.interval_s = 1.0
    cfg.tpu_compute_config.min_device_prefixes = 0  # always device
    cfg.parallel_config = ParallelConfig(min_shard_rows=0)
    cfg.resilience_config = ResilienceConfig(
        shadow_sample_every=SAMPLE_EVERY,
        failure_threshold=2,
        probe_backoff_initial_s=0.5,
        probe_backoff_max_s=4.0,
        jitter_pct=0.1,
        seed=7,
    )


async def _per_chip_corrupt_run():
    from openr_tpu.chaos import ChaosController, FaultPlan, InvariantChecker
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.emulation.topology import grid_edges

    clock = SimClock()
    net = EmulatedNetwork(
        clock, use_tpu_backend=True, config_overrides=_overrides
    )
    net.build(grid_edges(3))  # 9 nodes
    net.start()
    checker = InvariantChecker(net)
    plan = FaultPlan().tpu_corrupt(
        VICTIM, at=2.0, duration=14.0, device_index=BAD_CHIP
    )
    controller = ChaosController(net, plan, seed=7)

    await clock.run_for(18.0)
    ok, why = net.converged_full_mesh()
    assert ok, why
    victim = net.nodes[VICTIM]
    backend = victim.decision.backend
    gov = backend.governor
    assert gov is not None and not gov.quarantined
    assert backend.pool.size == 8  # the conftest's forced host devices
    # widen the candidate table so EVERY chip's shard holds at least two
    # real prefix rows (9 loopbacks over 8 chips would leave single-row
    # shards, and a shard holding only the victim's own self-skipped
    # prefix would make its corruption invisible by construction)
    net.nodes["node0"].advertise_prefixes(
        [PrefixEntry(f"10.99.{i}.0/24") for i in range(9)]
    )
    await clock.run_for(3.0)

    controller.start()
    await clock.run_for(3.0)  # corruption live at t=2 on chip 3 only
    # drive FULL rebuilds during the corrupt window (a link-down is a
    # topology change, so every node runs a sharded full build; a
    # DIFFERENT link each time — a refailed link whose adjacency never
    # re-formed would be a no-op publication).  Detection must land
    # within ONE shadow-sample interval of device builds.
    flapped = [("node0", "node1"), ("node1", "node2")][:SAMPLE_EVERY]
    for a, b in flapped:
        net.fail_link(a, b)
        await clock.run_for(2.0)
        checker.sample()
        if gov.num_shadow_mismatches:
            break
    assert gov.num_shadow_mismatches >= 1, (
        "per-chip silent corruption escaped shadow verification"
    )
    # ONLY chip k is quarantined: 7 survivors, node latch DOWN
    assert gov.num_chip_quarantines >= 1
    assert not backend.pool.is_healthy(BAD_CHIP)
    assert backend.pool.num_healthy == 7
    assert not backend.device_failed
    assert gov.num_quarantines == 0  # no whole-backend quarantine
    # ...so serving and what-if queries KEEP using the device engines
    assert victim.decision.device_available()
    summary = victim.decision.get_fleet_rib_summary()
    assert summary is not None and len(summary) == 9
    edges = [["node3", "node4"], ["node1", "node4"]]
    whatif = victim.decision.get_link_failure_whatif(edges)
    assert whatif is not None and whatif["eligible"]
    # the victim's FIB stays exact (scalar swap on the mismatch build,
    # survivor shards after): routes match a fresh oracle, no blackholes
    checker.check_no_blackholes()
    oracle = SpfSolver(VICTIM).build_route_db(
        victim.decision.area_link_states, victim.decision.prefix_state
    )
    assert norm_db(victim.decision.route_db) == norm_db(oracle)

    # restore the failed links and let the mesh re-converge (these full
    # rebuilds run on the 7 survivors; chip-3 probe shards that ride
    # them FAIL verification while the corruption is live, doubling its
    # backoff — recovery must wait for the heal)
    for a, b in flapped:
        net.restore_link(a, b)
    await clock.run_for(5.0)
    # heal fires at t=16 on the chaos clock (chaos requests a probe on
    # chip 3); drive one more full rebuild to carry the probe shard
    await clock.run_for(6.0)
    net.fail_link("node6", "node7")
    await clock.run_for(2.0)
    net.restore_link("node6", "node7")
    await clock.run_for(3.0)
    assert backend.pool.is_healthy(BAD_CHIP), (
        "chip not restored after heal + probe"
    )
    assert gov.num_chip_restores >= 1
    assert gov._chip_breaker(BAD_CHIP).num_probes >= 1
    assert not backend.device_failed

    await clock.run_for(8.0)
    checker.check_all()
    assert controller.done

    chaos_dump = controller.counter_dump()
    resilience_dump = victim.counters.dump("resilience.")
    assert (
        resilience_dump.get("resilience.backend.shadow_mismatches", 0) >= 1
    )
    await controller.stop()
    await net.stop()
    return chaos_dump, resilience_dump


@pytest.mark.chaos
def test_per_chip_corrupt_quarantine_survivors_serve_deterministic():
    a = run(_per_chip_corrupt_run())
    b = run(_per_chip_corrupt_run())
    # reproducibility contract: same seed => byte-identical dumps
    assert a == b
    chaos_dump, _ = a
    assert chaos_dump["chaos.injects"] == 1
    assert chaos_dump["chaos.heals"] == 1
    assert f"chaos.inject.tpu_corrupt.{BAD_CHIP}.{VICTIM}" in chaos_dump
