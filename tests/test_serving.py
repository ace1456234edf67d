"""Serving plane — micro-batching, content-addressed caching, admission.

The contract under test (docs/Serving.md):

* N concurrent distinct what-if queries against one LSDB generation are
  answered by EXACTLY ONE device batch solve (counter-verified on the
  engine), with per-request answers identical to the unbatched path;
* repeated queries hit the result cache and are served without ANY
  solve; a generation bump (LSDB churn or RibPolicy flip) invalidates;
* identical in-flight queries dedup onto one future;
* the bounded queue sheds (policy-selectable) instead of growing, token
  quotas refuse over-budget clients, and a TPU outage degrades the
  batcher to the scalar/native paths without deadlock.

All timing rides SimClock — every test replays deterministically.
"""

import asyncio

import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.config import DecisionConfig, ServingConfig
from openr_tpu.decision.backend import ScalarBackend, TpuBackend
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib_policy import (
    RibPolicy,
    RibPolicyStatement,
    RibRouteActionWeight,
)
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.serving import (
    QueryService,
    ServingQuotaError,
    ServingRejectedError,
    ServingShedError,
    canonical_query,
)
from openr_tpu.types import PrefixEntry

pytestmark = pytest.mark.serving


def build_decision(clock, backend_cls=TpuBackend, n_side=4):
    edges = grid_edges(n_side)
    dbs = build_adj_dbs(edges)
    ls = LinkState("0")
    for db in dbs.values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(n_side * n_side):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    solver = SpfSolver("node0")
    d = Decision(
        "node0",
        clock,
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=backend_cls(solver),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    d._change_seq = 1
    if backend_cls is TpuBackend:
        # deterministic engine choice: a zero dispatch round trip makes
        # the DEVICE what-if engine win the native-vs-device calibration
        d.backend.auto_dispatch_rt_ms = 0.0
    return d, edges


def make_serving(clock, d, **overrides):
    cfg = ServingConfig(**overrides)
    return QueryService(
        "node0", clock, cfg, d, counters=d.counters
    )


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        # tests leave the batcher fiber parked on its arrival event;
        # cancel stragglers so loop.close() is silent
        pending = asyncio.all_tasks(loop)
        for t in pending:
            t.cancel()
        if pending:
            loop.run_until_complete(
                asyncio.gather(*pending, return_exceptions=True)
            )
        loop.close()


async def settle(clock, duration=0.1):
    await clock.run_for(duration)


def norm_routes(db_wire: dict) -> dict:
    """Route-order-insensitive view of a RouteDatabase wire dict (the
    fleet decode emits prefix-sorted rows, the scalar solver insertion
    order; content must be identical)."""
    import json

    return {
        **db_wire,
        "unicast_routes": sorted(
            db_wire["unicast_routes"],
            key=lambda r: json.dumps(r, sort_keys=True, default=str),
        ),
        "mpls_routes": sorted(
            db_wire["mpls_routes"],
            key=lambda r: json.dumps(r, sort_keys=True, default=str),
        ),
    }


# ---------------------------------------------------------------------------
# micro-batching + dedup + cache
# ---------------------------------------------------------------------------


def test_eight_concurrent_whatif_queries_one_device_batch_solve():
    """THE acceptance bar: >=8 concurrent identical-generation what-if
    queries -> exactly 1 device batch solve, counter-verified, answers
    identical to the unbatched path; a second round is served from the
    cache without any solve."""

    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(clock, d)
        sv.start()
        pairs = [(a, b) for a, b, _m in edges][:8]
        # the unbatched oracle: one direct engine call per query (run
        # FIRST so its own engine counters don't pollute the assert;
        # use a dedicated Decision so the serving path's engines start
        # cold)
        oracle_d, _ = build_decision(clock)
        oracle = {
            p: oracle_d.get_link_failure_whatif([list(p)]) for p in pairs
        }

        tasks = [
            asyncio.ensure_future(
                sv.submit("whatif", {"link_failures": [p]})
            )
            for p in pairs
        ]
        await settle(clock)
        results = [t.result() for t in tasks]
        engine = d._whatif_engine
        assert engine is not None, "device what-if engine must serve this"
        assert engine.num_sweeps == 1, (
            "8 concurrent queries must coalesce into ONE device sweep"
        )
        assert sv.num_batches == 1
        assert d.counters.get("serving.batches") == 1
        for p, got in zip(pairs, results):
            want = oracle[p]
            assert got["eligible"] and want["eligible"]
            assert got["failures"] == want["failures"], p

        # round 2: pure cache hits — NO additional solve of any kind
        tasks = [
            asyncio.ensure_future(
                sv.submit("whatif", {"link_failures": [p]})
            )
            for p in pairs
        ]
        await settle(clock)
        cached = [t.result() for t in tasks]
        assert cached == results
        assert engine.num_sweeps == 1  # untouched
        assert sv.num_batches == 1  # no new batch either
        assert d.counters.get("serving.cache.hits") == 8

    run(main())


def test_identical_inflight_queries_dedup_onto_one_future():
    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(clock, d)
        sv.start()
        pair = (edges[0][0], edges[0][1])
        tasks = [
            asyncio.ensure_future(
                sv.submit("whatif", {"link_failures": [pair]})
            )
            for _ in range(4)
        ]
        await settle(clock)
        results = [t.result() for t in tasks]
        assert all(r == results[0] for r in results)
        assert sv.num_dedup_hits == 3
        assert d._whatif_engine.num_sweeps == 1

    run(main())


def test_route_db_batch_rides_one_fleet_solve():
    """A flush of K route_db queries costs ONE fleet batch solve + K
    decodes (the fleet engine's all-roots table), and each answer equals
    the scalar per-vantage oracle."""

    async def main():
        clock = SimClock()
        d, _edges = build_decision(clock)
        sv = make_serving(clock, d)
        sv.start()
        nodes = [f"node{i}" for i in range(8)]
        tasks = [
            asyncio.ensure_future(sv.submit("route_db", {"node": n}))
            for n in nodes
        ]
        await settle(clock)
        results = [t.result() for t in tasks]
        fleet = d._fleet_engine
        assert fleet is not None and fleet.num_batched_solves == 1
        assert fleet.num_decodes == 8
        for n, got in zip(nodes, results):
            oracle = (
                SpfSolver(n)
                .build_route_db(d.area_link_states, d.prefix_state)
                .to_route_database(n)
                .to_wire()
            )
            assert norm_routes(got) == norm_routes(oracle), n

    run(main())


def test_max_batch_flushes_without_waiting_for_timer():
    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(clock, d, max_batch=4, max_wait_ms=60_000)
        sv.start()
        pairs = [(a, b) for a, b, _m in edges][:4]
        tasks = [
            asyncio.ensure_future(
                sv.submit("whatif", {"link_failures": [p]})
            )
            for p in pairs
        ]
        # virtually no time passes: the full batch must flush on count
        await settle(clock, 0.001)
        assert all(t.done() for t in tasks)
        assert sv.num_batches == 1

    run(main())


# ---------------------------------------------------------------------------
# cache invalidation: generation = (LSDB, RibPolicy)
# ---------------------------------------------------------------------------


def _weight_policy(clock) -> RibPolicy:
    return RibPolicy(
        statements=[
            RibPolicyStatement(
                name="t",
                prefixes=["10.1.0.0/24"],
                action=RibRouteActionWeight(default_weight=3),
            )
        ],
        valid_until=clock.now() + 3600.0,
    )


def test_policy_flip_invalidates_serving_cache_and_fleet_cache():
    """Satellite regression: a RibPolicy set/clear between two
    identical-LSDB queries MUST invalidate the fleet table cache and the
    serving result cache (generation is (LSDB, policy), not LSDB)."""

    async def main():
        clock = SimClock()
        d, _edges = build_decision(clock)
        sv = make_serving(clock, d)
        sv.start()

        async def one_query():
            return await asyncio.ensure_future(
                sv.submit("route_db", {"node": "node3"})
            )

        t = asyncio.ensure_future(one_query())
        await settle(clock)
        t.result()
        fleet = d._fleet_engine
        assert fleet.num_batched_solves == 1
        gen_before = d.generation_key()

        d.set_rib_policy(_weight_policy(clock))
        assert d.generation_key() != gen_before
        # eager invalidation ran (rebuild-path hook)
        assert len(sv.cache) == 0
        assert d.counters.get("serving.cache.generation_invalidations") >= 1

        t = asyncio.ensure_future(one_query())
        await settle(clock)
        t.result()
        # identical LSDB, but the policy flip forced a re-solve
        assert fleet.num_batched_solves == 2
        assert d.counters.get("serving.cache.hits") == 0

        d.clear_rib_policy()
        t = asyncio.ensure_future(one_query())
        await settle(clock)
        t.result()
        assert fleet.num_batched_solves == 3

    run(main())


def test_fleet_cache_policy_flip_regression_direct():
    """The same satellite regression WITHOUT the serving plane: two
    identical-LSDB compute_route_db_for_node calls around a policy flip
    re-solve the fleet tables instead of serving the stale cache."""
    clock = SimClock()
    d, _edges = build_decision(clock)
    d.compute_route_db_for_node("node5")
    assert d._fleet_engine.num_batched_solves == 1
    d.compute_route_db_for_node("node5")
    assert d._fleet_engine.num_batched_solves == 1  # cached
    d.set_rib_policy(_weight_policy(clock))
    d.compute_route_db_for_node("node5")
    assert d._fleet_engine.num_batched_solves == 2  # policy flip re-solved
    d.clear_rib_policy()
    d.compute_route_db_for_node("node5")
    assert d._fleet_engine.num_batched_solves == 3


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_reject_newest_when_queue_full():
    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(
            clock, d, max_queue_depth=2, max_batch=64, max_wait_ms=50
        )
        sv.start()
        pairs = [(a, b) for a, b, _m in edges][:3]
        t1 = asyncio.ensure_future(
            sv.submit("whatif", {"link_failures": [pairs[0]]})
        )
        t2 = asyncio.ensure_future(
            sv.submit("whatif", {"link_failures": [pairs[1]]})
        )
        t3 = asyncio.ensure_future(
            sv.submit("whatif", {"link_failures": [pairs[2]]})
        )
        await settle(clock, 0.2)
        assert t1.result()["eligible"] and t2.result()["eligible"]
        with pytest.raises(ServingRejectedError):
            t3.result()
        assert sv.num_rejected == 1

    run(main())


def test_shed_oldest_evicts_longest_waiter():
    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(
            clock, d, max_queue_depth=2, max_batch=64, max_wait_ms=50,
            shed_policy="shed_oldest",
        )
        sv.start()
        pairs = [(a, b) for a, b, _m in edges][:3]
        tasks = [
            asyncio.ensure_future(
                sv.submit("whatif", {"link_failures": [p]})
            )
            for p in pairs
        ]
        await settle(clock, 0.2)
        with pytest.raises(ServingShedError):
            tasks[0].result()  # the OLDEST was shed in the newest's favor
        assert tasks[1].result()["eligible"]
        assert tasks[2].result()["eligible"]
        assert sv.num_shed == 1
        assert d.counters.get("serving.shed") == 1

    run(main())


def test_client_token_quota_refuses_and_refills():
    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(
            clock, d, quota_tokens=2, quota_refill_per_s=1.0
        )
        sv.start()
        pairs = [(a, b) for a, b, _m in edges]

        async def q(i, client):
            return await sv.submit(
                "whatif", {"link_failures": [pairs[i]]}, client_id=client
            )

        t1 = asyncio.ensure_future(q(0, "alice"))
        t2 = asyncio.ensure_future(q(1, "alice"))
        t3 = asyncio.ensure_future(q(2, "alice"))
        t4 = asyncio.ensure_future(q(3, "bob"))  # separate bucket
        await settle(clock, 0.2)
        assert t1.result()["eligible"] and t2.result()["eligible"]
        with pytest.raises(ServingQuotaError):
            t3.result()
        assert t4.result()["eligible"]
        assert sv.num_quota_rejected == 1
        # tokens refill on the injected clock: 2 virtual seconds -> 2
        await settle(clock, 2.0)
        t5 = asyncio.ensure_future(q(4, "alice"))
        await settle(clock, 0.2)
        assert t5.result()["eligible"]

    run(main())


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------


def test_canonical_query_normalizes_pair_order():
    a = canonical_query(
        "whatif", {"link_failures": [("node1", "node2")]}
    )
    b = canonical_query(
        "whatif", {"link_failures": [("node2", "node1")]}
    )
    assert a == b
    # simultaneous sets ignore listing order entirely
    s1 = canonical_query(
        "whatif",
        {"link_failures": [("a", "b"), ("c", "d")], "simultaneous": True},
    )
    s2 = canonical_query(
        "whatif",
        {"link_failures": [("d", "c"), ("b", "a")], "simultaneous": True},
    )
    assert s1 == s2
    # ...but per-failure queries preserve response row order
    o1 = canonical_query(
        "whatif", {"link_failures": [("a", "b"), ("c", "d")]}
    )
    o2 = canonical_query(
        "whatif", {"link_failures": [("c", "d"), ("a", "b")]}
    )
    assert o1 != o2


def test_trace_spans_chain_enqueue_batch_solve_kernel():
    """A served query renders as serving.enqueue → serving.batch_solve
    → decision.spf_kernel spans in one trace (the Observability.md
    catalogue), and the queue-wait/batch-size histograms observe."""

    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        from openr_tpu.tracing import Tracer

        tracer = Tracer("node0", clock, counters=d.counters)
        sv = QueryService(
            "node0", clock, ServingConfig(), d,
            counters=d.counters, tracer=tracer,
        )
        sv.start()
        pair = (edges[0][0], edges[0][1])
        t = asyncio.ensure_future(
            sv.submit("whatif", {"link_failures": [pair]})
        )
        await settle(clock)
        assert t.result()["eligible"]
        by_name: dict = {}
        for s in tracer.get_spans():
            by_name.setdefault(s.name, []).append(s)
        enq = by_name["serving.enqueue"][0]
        solve = by_name["serving.batch_solve"][0]
        assert solve.parent_id == enq.span_id
        assert solve.trace_id == enq.trace_id
        assert solve.attrs["batch_size"] == 1
        kernels = by_name.get("decision.spf_kernel", [])
        assert any(
            k.parent_id == solve.span_id and k.trace_id == enq.trace_id
            for k in kernels
        ), "kernel dispatches must parent under the batch solve"
        for key in ("serving.queue_wait_ms", "serving.batch_size",
                    "serving.batch_solve_ms"):
            h = d.counters.histogram(key)
            assert h is not None and h.count >= 1, key

    run(main())


def test_disabled_serving_answers_inline():
    """serving_config.enabled=false: no batcher fiber runs, but the
    verbs still answer (inline, unbatched) — flipping the knob never
    strands a client."""

    async def main():
        clock = SimClock()
        d, edges = build_decision(clock)
        sv = make_serving(clock, d, enabled=False)
        # deliberately NOT started: disabled mode must not need the fiber
        pair = (edges[0][0], edges[0][1])
        got = await sv.submit("whatif", {"link_failures": [pair]})
        assert got["eligible"]
        db = await sv.submit("route_db", {"node": "node1"})
        assert db["this_node_name"] == "node1"
        assert sv.num_batches == 0
        # still cached: the second identical query is a hit
        again = await sv.submit("whatif", {"link_failures": [pair]})
        assert again == got
        assert d.counters.get("serving.cache.hits") == 1

    run(main())


def test_scalar_backend_serving_still_works():
    """The serving plane is not a device feature: scalar deployments
    batch/cache/shed the same way over the scalar engines."""

    async def main():
        clock = SimClock()
        d, _edges = build_decision(clock, backend_cls=ScalarBackend)
        sv = make_serving(clock, d)
        sv.start()
        t = asyncio.ensure_future(sv.submit("route_db", {"node": "node2"}))
        await settle(clock)
        got = t.result()
        oracle = (
            SpfSolver("node2")
            .build_route_db(d.area_link_states, d.prefix_state)
            .to_route_database("node2")
            .to_wire()
        )
        assert norm_routes(got) == norm_routes(oracle)

    run(main())
