"""Fib — route programming with retries, sync, and dryrun.

Reference: openr/fib/Fib.{h,cpp}: consumes DecisionRouteUpdates, programs
them into a FibService agent (thrift to the platform in the reference; an
abstract FibAgent here — Mock in-memory, Netlink via openr_tpu.platform,
or dryrun log-only Fib.h:352), with:
  * ordered programming: adds/updates immediately, deletes delayed by
    route_delete_delay_ms (default 1s) to let penultimate hops reroute
  * retry with exponential backoff on agent failure (retryRoutesTask,
    Fib.cpp:983; Constants.h:81-82 8ms→4096ms)
  * agent keepalive: aliveSince regression → full syncRoutes
    (keepAliveTask, Fib.cpp:1057)
  * publishes programmed deltas on fibRouteUpdatesQueue → PrefixManager
  * streams updates to subscribers (ctrl surface)
  * FIB_SYNCED initialization event after the first successful sync
"""

from __future__ import annotations

import asyncio
import collections
from typing import Callable, Deque, Dict, List, Optional

from openr_tpu import constants as C
from openr_tpu.common.runtime import Actor, Clock, CounterMap
from openr_tpu.common.utils import ExponentialBackoff
from openr_tpu.config import FibConfig
from openr_tpu.decision.rib import (
    DecisionRouteUpdate,
    DecisionRouteUpdateType,
    RibMplsEntry,
    RibUnicastEntry,
)
from openr_tpu.messaging.queue import RQueue, ReplicateQueue
from openr_tpu.resilience import STATE_CLOSED, CircuitBreaker
from openr_tpu.tracing import pipeline
from openr_tpu.types import InitializationEvent, MplsRoute, PerfEvents, UnicastRoute


class FibAgentError(RuntimeError):
    pass


class FibAgent:
    """Agent API (thrift FibService equivalent, if/Platform.thrift:78-160)."""

    async def add_unicast_routes(self, routes: List[UnicastRoute]) -> None:
        raise NotImplementedError

    async def delete_unicast_routes(self, prefixes: List[str]) -> None:
        raise NotImplementedError

    async def add_mpls_routes(self, routes: List[MplsRoute]) -> None:
        raise NotImplementedError

    async def delete_mpls_routes(self, labels: List[int]) -> None:
        raise NotImplementedError

    async def sync_fib(
        self, routes: List[UnicastRoute], mpls_routes: List[MplsRoute]
    ) -> None:
        raise NotImplementedError

    async def alive_since(self) -> float:
        raise NotImplementedError


class MockFibAgent(FibAgent):
    """In-memory agent (tests/mocks/MockNetlinkFibHandler.h pattern):
    holds programmed state, supports failure injection and restart
    simulation."""

    def __init__(self, clock: Clock) -> None:
        self.clock = clock
        self.unicast: Dict[str, UnicastRoute] = {}
        self.mpls: Dict[int, MplsRoute] = {}
        self._alive_since = clock.now()
        self.fail = False
        self.num_sync = 0
        self.num_add = 0
        self.num_del = 0

    def _check(self) -> None:
        if self.fail:
            raise FibAgentError("injected agent failure")

    async def add_unicast_routes(self, routes: List[UnicastRoute]) -> None:
        self._check()
        self.num_add += len(routes)
        for r in routes:
            self.unicast[r.dest] = r

    async def delete_unicast_routes(self, prefixes: List[str]) -> None:
        self._check()
        self.num_del += len(prefixes)
        for p in prefixes:
            self.unicast.pop(p, None)

    async def add_mpls_routes(self, routes: List[MplsRoute]) -> None:
        self._check()
        for r in routes:
            self.mpls[r.top_label] = r

    async def delete_mpls_routes(self, labels: List[int]) -> None:
        self._check()
        for label in labels:
            self.mpls.pop(label, None)

    async def sync_fib(self, routes, mpls_routes) -> None:
        self._check()
        self.num_sync += 1
        self.unicast = {r.dest: r for r in routes}
        self.mpls = {r.top_label: r for r in mpls_routes}

    async def alive_since(self) -> float:
        self._check()
        return self._alive_since

    def restart(self) -> None:
        """Simulate agent restart: programmed state lost, aliveSince bumps."""
        self.unicast.clear()
        self.mpls.clear()
        self._alive_since = self.clock.now()


class Fib(Actor):
    def __init__(
        self,
        node_name: str,
        clock: Clock,
        config: FibConfig,
        agent: Optional[FibAgent],
        route_updates_reader: RQueue,
        fib_route_updates_queue: Optional[ReplicateQueue] = None,
        initialization_cb: Optional[Callable[[InitializationEvent], None]] = None,
        counters: Optional[CounterMap] = None,
        dryrun: bool = False,
        tracer=None,
        probe=None,
    ) -> None:
        super().__init__("fib", clock, counters)
        from openr_tpu.tracing import disabled_tracer

        self.tracer = tracer if tracer is not None else disabled_tracer()
        #: the node's PipelineProbe (Decision's): the RIB apply and route
        #: conversions of each update record ``fib_program``; the agent
        #: calls, another process in a deployment, are left out of it
        self.probe = probe if probe is not None else pipeline.disabled_probe()
        self.node_name = node_name
        self.config = config
        self.agent = agent
        self.dryrun = dryrun or agent is None
        self.route_updates_reader = route_updates_reader
        self.fib_route_updates_queue = fib_route_updates_queue
        self.initialization_cb = initialization_cb
        #: authoritative desired state (routeState_ in Fib.h)
        self.unicast_routes: Dict[str, RibUnicastEntry] = {}
        self.mpls_routes: Dict[int, RibMplsEntry] = {}
        self._dirty = False  # programming failed; retry pending
        self._backoff = ExponentialBackoff(
            C.FIB_INITIAL_BACKOFF_S, C.FIB_MAX_BACKOFF_S, clock
        )
        #: agent-session circuit breaker (openr_tpu.resilience),
        #: augmenting the raw backoff above: the FIRST agent failure
        #: opens it, so incremental programming and delayed deletes
        #: short-circuit to dirty instead of hammering a failing agent
        #: with per-update RPCs — the retry fiber's full syncs are the
        #: half-open probes that close it.  Retry CADENCE stays on
        #: `_backoff` (unchanged semantics); the breaker contributes the
        #: shared state machine + the `resilience.fib_agent.*` gauges.
        import zlib

        self.breaker = CircuitBreaker(
            "fib_agent",
            clock,
            failure_threshold=1,
            backoff_initial_s=C.FIB_INITIAL_BACKOFF_S,
            backoff_max_s=C.FIB_MAX_BACKOFF_S,
            jitter_pct=0.1,
            seed=zlib.crc32(node_name.encode()),
            counters=self.counters,
        )
        self.num_retries = 0
        self._synced = False
        self._agent_alive_since: Optional[float] = None
        self._retry_wakeup: Optional[asyncio.Future] = None
        #: convergence breadcrumb history, newest last (reference keeps a
        #: kPerfBufferSize=10 ring exposed via getPerfDb,
        #: Constants.h:204-208, if/OpenrCtrl.thrift:465)
        self.perf_db: Deque[PerfEvents] = collections.deque(
            maxlen=C.PERF_BUFFER_SIZE
        )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self.spawn_queue_loop(
            self.route_updates_reader, self._on_route_update, "fib.routes"
        )
        if not self.dryrun:
            self.spawn(self._keepalive_loop(), name="fib.keepalive")
            self.spawn(self._retry_loop(), name="fib.retry")

    # -- route update processing (processDecisionRouteUpdate) --------------

    async def _on_route_update(self, update: DecisionRouteUpdate) -> None:
        span = self.tracer.start_span(
            "fib.program",
            update.trace_ctx,
            module="fib",
            routes=update.size(),
            sync=update.type == DecisionRouteUpdateType.FULL_SYNC,
        )
        try:
            await self._process_route_update(update)
        finally:
            self.tracer.end_span(span, synced=not self._dirty)
            if update.frr:
                self.counters.bump("fib.frr_patches_applied")
            ctx = update.trace_ctx
            if ctx is not None:
                # trace closes here: programming acknowledged (or marked
                # dirty for retry).  Event→FIB latency is measured from
                # the ORIGIN's clock stamp, so a multi-node trace reports
                # true cross-node convergence (nodes share the SimClock
                # in emulation; wall-clock deployments inherit host skew).
                self.counters.observe(
                    "convergence.event_to_fib_ms",
                    max(self.clock.now_ms() - ctx.t0_ms, 0),
                )
                if update.frr:
                    # protection-tier fast path: the same event→FIB
                    # latency, broken out so the bench can compare the
                    # patched path against the warm-solve path
                    self.counters.observe(
                        "convergence.frr_event_to_fib_ms",
                        max(self.clock.now_ms() - ctx.t0_ms, 0),
                    )
                self.tracer.instant(
                    "fib.ack",
                    self.tracer.child_ctx(span, ctx),
                    module="fib",
                    origin=ctx.origin_event,
                    origin_node=ctx.origin_node,
                    dirty=self._dirty,
                )

    async def _process_route_update(self, update: DecisionRouteUpdate) -> None:
        full_sync = update.type == DecisionRouteUpdateType.FULL_SYNC
        with self.probe.phase(pipeline.FIB_PROGRAM):
            if full_sync:
                self.unicast_routes = dict(update.unicast_routes_to_update)
                self.mpls_routes = dict(update.mpls_routes_to_update)
            else:
                self._apply_incremental(update)
        if full_sync:
            await self._sync_routes()
        else:
            await self._program_incremental(update)
        # notify PrefixManager et al of (intended-as-)programmed routes
        if self.fib_route_updates_queue is not None:
            self.fib_route_updates_queue.push(update)
        if update.perf_events is not None:
            update.perf_events.add(
                self.node_name, "FIB_ROUTES_PROGRAMMED", self.clock.now_ms()
            )
            self.counters.set(
                "fib.convergence_time_ms", update.perf_events.total_duration_ms()
            )
            self.perf_db.append(update.perf_events)

    def _apply_incremental(self, update: DecisionRouteUpdate) -> None:
        for prefix, entry in update.unicast_routes_to_update.items():
            prior = self.unicast_routes.get(prefix)
            self.unicast_routes[prefix] = entry
            if (
                entry.do_not_install
                and prior is not None
                and not prior.do_not_install
            ):
                # installed route flipped to do_not_install: withdraw it
                update.unicast_routes_to_delete.append(prefix)
        for prefix in update.unicast_routes_to_delete:
            if prefix not in update.unicast_routes_to_update:
                self.unicast_routes.pop(prefix, None)
        for label, mentry in update.mpls_routes_to_update.items():
            self.mpls_routes[label] = mentry
        for label in update.mpls_routes_to_delete:
            self.mpls_routes.pop(label, None)

    def get_perf_db(self) -> List[PerfEvents]:
        """ctrl API getPerfDb (if/OpenrCtrl.thrift:465)."""
        return list(self.perf_db)

    async def _program_incremental(self, update: DecisionRouteUpdate) -> None:
        if self.dryrun:
            self.counters.bump("fib.dryrun_updates")
            self._mark_synced()
            return
        if not self.breaker.allow_request():
            # open breaker: the agent just failed — don't pay it another
            # per-update RPC; mark dirty and let the retry fiber's full
            # sync probe it on the backoff schedule
            self._mark_dirty(agent_failed=False)
            return
        with self.probe.phase(pipeline.FIB_PROGRAM):
            adds = [
                e.to_unicast_route()
                for e in update.unicast_routes_to_update.values()
                if not e.do_not_install
            ]
            mpls_adds = [
                e.to_mpls_route()
                for e in update.mpls_routes_to_update.values()
            ]
        try:
            if adds:
                await self.agent.add_unicast_routes(adds)
            if mpls_adds:
                await self.agent.add_mpls_routes(mpls_adds)
            # deletes are delayed to let the network reroute first
            # (route_delete_delay_ms, OpenrConfig default 1s)
            if update.unicast_routes_to_delete or update.mpls_routes_to_delete:
                self.schedule(
                    self.config.route_delete_delay_ms / 1000.0,
                    lambda u=update: self._delayed_delete(u),
                )
            self._backoff.report_success()
            self.breaker.record_success()
            self._mark_synced()
        except FibAgentError:
            self._mark_dirty()

    def _delayed_delete(self, update: DecisionRouteUpdate):
        async def _run():
            if not self.breaker.allow_request():
                self._mark_dirty(agent_failed=False)
                return
            try:
                # skip deletes that were re-added as installable meanwhile
                def still_wanted(p):
                    e = self.unicast_routes.get(p)
                    return e is not None and not e.do_not_install

                dels = [
                    p
                    for p in update.unicast_routes_to_delete
                    if not still_wanted(p)
                ]
                did_rpc = False
                if dels:
                    await self.agent.delete_unicast_routes(dels)
                    did_rpc = True
                mdels = [
                    l
                    for l in update.mpls_routes_to_delete
                    if l not in self.mpls_routes
                ]
                if mdels:
                    await self.agent.delete_mpls_routes(mdels)
                    did_rpc = True
                if did_rpc:
                    self.breaker.record_success()
                else:
                    # nothing left to delete: the agent was never
                    # exercised — release an acquired probe unscored
                    self.breaker.release_probe()
            except FibAgentError:
                self._mark_dirty()

        return _run()

    async def _sync_routes(self) -> None:
        """Full state sync (syncRoutes, Fib.cpp:847)."""
        if self.dryrun:
            self.counters.bump("fib.dryrun_syncs")
            self._mark_synced()
            return
        with self.probe.phase(pipeline.FIB_PROGRAM):
            routes = [
                e.to_unicast_route()
                for e in self.unicast_routes.values()
                if not e.do_not_install
            ]
            mpls_routes = [e.to_mpls_route() for e in self.mpls_routes.values()]
        try:
            await self.agent.sync_fib(routes, mpls_routes)
            self._backoff.report_success()
            self.breaker.record_success()
            self.counters.bump("fib.num_sync")
            self._mark_synced()
        except FibAgentError:
            self._mark_dirty()

    def _mark_synced(self) -> None:
        self._dirty = False
        self.counters.set(
            "fib.backoff_ms", self._backoff.get_current_backoff() * 1000.0
        )
        if not self._synced:
            self._synced = True
            if self.initialization_cb is not None:
                self.initialization_cb(InitializationEvent.FIB_SYNCED)

    def _mark_dirty(self, agent_failed: bool = True) -> None:
        self._dirty = True
        self._backoff.report_error()
        if agent_failed:
            # score the breaker only on OBSERVED agent failures — a
            # short-circuited attempt (breaker already open) is not new
            # evidence against the agent
            self.breaker.record_failure()
        self.counters.bump("fib.programming_failures")
        self.counters.set(
            "fib.backoff_ms", self._backoff.get_current_backoff() * 1000.0
        )
        if self._retry_wakeup is not None and not self._retry_wakeup.done():
            self._retry_wakeup.set_result(None)

    # -- retry fiber (retryRoutesTask, Fib.cpp:983) ------------------------

    async def _retry_loop(self) -> None:
        while True:
            if not self._dirty:
                self._retry_wakeup = asyncio.get_running_loop().create_future()
                await self._retry_wakeup
            await self.clock.sleep(self._backoff.get_current_backoff())
            if self._dirty:
                self.num_retries += 1
                self.counters.bump("fib.retries")
                # this retry IS the half-open probe when the hold has
                # elapsed (cadence stays on `_backoff`; the breaker only
                # scores outcomes so its hold ladder tracks failed probes)
                if (
                    self.breaker.state != STATE_CLOSED
                    and self.breaker.time_until_probe_s() <= 0
                ):
                    self.breaker.allow_request()
                await self._sync_routes()

    def retry_state(self) -> Dict[str, float]:
        """Gauge snapshot for the Monitor's provider sweep: retry count,
        live backoff, and dirty/synced flags — the signals a chaos run (or
        an operator via `breeze monitor counters fib.`) watches to confirm
        the agent-retry machinery is actually exercising."""
        out = {
            "fib.retries": float(self.num_retries),
            "fib.backoff_ms": self._backoff.get_current_backoff() * 1000.0,
            "fib.dirty": 1.0 if self._dirty else 0.0,
            "fib.synced": 1.0 if self._synced else 0.0,
        }
        # shared resilience gauge schema (resilience.fib_agent.*): same
        # shape as the device governor's and the kv transport's breakers
        out.update(self.breaker.counter_snapshot())
        return out

    # -- agent keepalive (keepAliveTask, Fib.cpp:1057) ---------------------

    async def _keepalive_loop(self) -> None:
        while True:
            await self.clock.sleep(C.KEEP_ALIVE_CHECK_INTERVAL_S)
            try:
                alive = await self.agent.alive_since()
            except FibAgentError:
                continue
            if self._agent_alive_since is None:
                self._agent_alive_since = alive
            elif alive != self._agent_alive_since:
                # agent restarted: it lost all programmed state
                self._agent_alive_since = alive
                self.counters.bump("fib.agent_restarts")
                await self._sync_routes()

    # -- ctrl surface ------------------------------------------------------

    def get_route_db(self) -> Dict[str, RibUnicastEntry]:
        return dict(self.unicast_routes)

    def get_mpls_route_db(self) -> Dict[int, RibMplsEntry]:
        return dict(self.mpls_routes)

    def get_unicast_routes_filtered(self, prefixes: List[str]) -> List[UnicastRoute]:
        if not prefixes:
            return [e.to_unicast_route() for e in self.unicast_routes.values()]
        return [
            e.to_unicast_route()
            for p, e in self.unicast_routes.items()
            if p in prefixes
        ]

    @property
    def synced(self) -> bool:
        return self._synced
